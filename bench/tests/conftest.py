"""Fixtures of the benchmark's own tests: a checkout-like root holding the
real ``bench/`` and ``src/`` beside a small configuration of each kind."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# 4^3 B20 cells (512 atoms): the smallest box whose linked-cell grid is 3
# cells wide; its cells hold about 19 atoms, so the cell capacity is raised
SMALL = {"cells": 4, "neighbor": {"capacity": 72, "skin": 0.5,
                                  "cell_capacity": 40}}
SHORT = {"episode_steps": 20, "chunk_steps": 10}
# 5^3 B20 cells (1,000 atoms): the smallest cubic box whose cell grid the
# Sharded plan splits over a 2x2 mesh (four cells a side, two a device);
# the plan resolves its grid and cell capacity itself
SHARDED = {"cells": 5, "plan": {"kind": "sharded",
                                "mesh": {"sx": 2, "sy": 2},
                                "axis_map": ["sx", "sy", None]}}


def _small_root(path, bench: dict) -> str:
    """A root at ``path`` with ``bench/`` copied, the given BENCHMARK.json,
    and small copies of the configurations and traffic under their names."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(path, "src"))
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(SMALL)
        with open(os.path.join(path, c["file"]), "w") as f:
            json.dump(cfg, f)
    for name in {w["traffic"] for w in bench["workloads"]}:
        p = os.path.join(path, "bench", "traffic", f"{name}.json")
        with open(p) as f:
            traffic = json.load(f)
        traffic.update(SHORT)
        with open(p, "w") as f:
            json.dump(traffic, f)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(path)


@pytest.fixture
def small_root(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return _small_root(tmp_path, bench)


def sharded_root(path, config: str, traffic: dict | None = None) -> str:
    """A root at ``path`` whose one cell, ``<config>-2x2``, runs a small
    copy of ``config`` on a 2x2 ``Sharded`` plan over four chips, with
    :data:`SHORT` episodes updated by ``traffic``, and the limits of
    ``config``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = dict([c for c in bench["configs"] if c["name"] == config][0])
    name = f"{config}-2x2"
    entry.update(name=name, file=f"bench/configs/{name}.json")
    bench["configs"] = [entry]
    bench["workloads"] = [{"name": name, "config": name,
                           "traffic": "fc-hold-ep100", "chips": 4,
                           "why": "test"}]
    root = _small_root(path, {**bench, "configs": []})
    with open(os.path.join(REPO, "bench", "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg.update(SMALL, name=name, **SHARDED)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "bench", "limits", f"{config}.json"),
                os.path.join(root, "bench", "limits", f"{name}.json"))
    p = os.path.join(root, "bench", "traffic", "fc-hold-ep100.json")
    with open(p) as f:
        mix = json.load(f)
    mix.update(traffic or {})
    with open(p, "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
