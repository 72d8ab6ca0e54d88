"""The harness on a ``Sharded`` plan: a cell whose configuration names a
2x2 mesh runs on a CPU host forced to four devices (a process of its own,
:mod:`bench.tests.sharded_run`) and comes out correct through an in-scan
rebuild that migrates atoms between cells and devices, with nothing
compiled in its window.  The NEP kernel path is left out: at this size
one such run takes over two minutes on the CPU."""
import json
import os

import pytest

from bench import check, harness
from bench.tests.conftest import sharded_root
from bench.tests.sharded_run import run

LONG = {"episode_steps": 60, "chunk_steps": 10}   # long enough to rebuild


def test_sharded_cell_is_correct_through_a_migration(tmp_path):
    root = sharded_root(tmp_path, "fege-heisenberg-dmi", LONG)
    res = run(root, "fege-heisenberg-dmi-2x2")
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["window"]["compiles"] == 0
    assert res["window"]["rebuilds"] >= 1
    assert set(res["checks"]) == set(check.limits(root,
                                                  "fege-heisenberg-dmi-2x2"))


@pytest.mark.parametrize("chips", [1, 2])
def test_cell_whose_mesh_differs_from_its_chips_is_refused(tmp_path, chips):
    root = sharded_root(tmp_path, "fege-heisenberg-dmi")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"][0]["chips"] = chips
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(ValueError, match="plan spans 4"):
        harness.load_cell(root, "fege-heisenberg-dmi-2x2")
