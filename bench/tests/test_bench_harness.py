"""The harness end to end on the CPU at a small size (the chip check is
skipped through ``require_accelerator=False``)."""
import json
import os
import shutil
import subprocess
import sys

from bench import check, harness
from bench.tests.conftest import REPO

SEED = 3_000_000_019    # wider than 32 bits, as the driver's seeds are


def _run(root, workload, traced=False, seconds=1.0):
    return harness.run_cell(root, workload, SEED, seconds, traced,
                            require_accelerator=False)


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "heis-fc-64k",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_without_result():
    out = _cli(REPO)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_small_run_is_correct_and_well_formed(small_root):
    rc, res = _run(small_root, "heis-fc-64k")
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"atom_steps_per_s_per_chip", "setup_s"}
    assert res["metrics"]["atom_steps_per_s_per_chip"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    lim = check.limits(small_root, "fege-heisenberg-dmi")
    assert set(res["checks"]) == set(lim)
    json.dumps(res)


def test_traced_run_reads_the_per_layer_metrics(small_root):
    rc, res = _run(small_root, "heis-fc-64k", traced=True)
    assert rc == 0 and res["correct"] is True
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    names = set(res["metrics"])
    assert {"device_idle_share", "compiles_in_window", "rebuilds_per_kstep",
            "force_ms_per_step"} <= names
    assert not names & {"nep_force_roofline", "nep_step_mfu"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_added_files_are_picked_up(small_root):
    """A configuration, a traffic mix and a per-layer metric added only as
    new files and entries of BENCHMARK.json run without any other edit."""
    root = small_root
    with open(os.path.join(root, "bench", "configs",
                           "fege-heisenberg-dmi.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "fege-heisenberg-weak-dmi"
    cfg["params"]["d0"] = 3.5e-4
    with open(os.path.join(root, "bench", "configs",
                           "fege-heisenberg-weak-dmi.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "bench", "limits",
                             "fege-heisenberg-dmi.json"),
                os.path.join(root, "bench", "limits",
                             "fege-heisenberg-weak-dmi.json"))
    with open(os.path.join(root, "bench", "traffic",
                           "fc-hold-ep100.json")) as f:
        traffic = json.load(f)
    traffic.update(name="cold-hold-ep10", episode_steps=10, chunk_steps=5)
    traffic["schedule"].update(t_hot_K=50.0, t_cold_K=20.0)
    with open(os.path.join(root, "bench", "traffic",
                           "cold-hold-ep10.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "bench", "metrics",
                           "episodes_per_s.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx['run']['restarts'] / ctx['run']['window_s']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": cfg["name"], "source": cfg["source"], "reduced": ["cells"],
        "file": "bench/configs/fege-heisenberg-weak-dmi.json", "why": "test"})
    bench["workloads"].append({
        "name": "weak-cold", "config": cfg["name"],
        "traffic": "cold-hold-ep10", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "episodes_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "host chunk boundary",
        "moves": "atom_steps_per_s_per_chip", "workloads": ["weak-cold"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, res = _run(root, "weak-cold", traced=True)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["metrics"]["episodes_per_s"]["value"] > 0
    rc, res = _run(root, "heis-fc-64k", traced=True)
    assert "episodes_per_s" not in res["metrics"]
