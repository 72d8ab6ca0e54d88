"""One run of one benchmark cell: set-up, the measured window, the
comparison with the plain references, and the result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name in files of its own:

* ``BENCHMARK.json`` names the cell's configuration file and traffic; the
  configuration may name the program's plan and its mesh, which spans as
  many chips as the cell asks for (:mod:`bench.builders.system`);
* ``bench/traffic/<traffic>.json`` holds the mix's parameters;
* ``bench/builders/<kind>.py`` builds the program's potential for a
  configuration's ``kind``, and ``bench/reference/<kind>.py`` its plain
  reference energy;
* ``bench/limits/<config>.json`` holds the limits of the comparison;
* ``bench/metrics/<metric>.py`` reads one metric from the run record and
  the reduced trace (``read(ctx)``, ``None`` when there is nothing to read).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from bench.trace import WINDOW

UNSUPPORTED = 2   # exit code: no accelerator, or fewer chips than the cell asks



@dataclasses.dataclass
class Cell:
    root: str
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]


def load_module(path: str, name: str):
    """Import a module of the benchmark from its file."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    wl = wl[0]
    entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    config = _read(os.path.join(root, entry["file"]))
    traffic = _read(os.path.join(root, "bench", "traffic",
                                 f"{wl['traffic']}.json"))
    plan = config.get("plan")
    span = math.prod(plan["mesh"].values()) if plan else 1
    if span != int(wl["chips"]):
        raise ValueError(f"cell {workload} asks for {wl['chips']} chip(s) "
                         f"and its configuration's plan spans {span}")
    return Cell(root, bench, wl, config, traffic)


def builder(cell: Cell):
    kind = cell.config["kind"]
    return load_module(os.path.join(cell.root, "bench", "builders",
                                    f"{kind}.py"), f"bench_builder_{kind}")


def metrics_of(cell: Cell, traced: bool) -> list[dict]:
    """The cell's metrics: end-to-end without the trace, per-layer with it."""
    group = cell.bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if "workloads" not in m
            or cell.name in m["workloads"]]


def read_metrics(cell: Cell, ctx: dict, traced: bool) -> dict:
    """Run each metric's reader; a reader that finds nothing is left out."""
    out = {}
    for m in metrics_of(cell, traced):
        mod = load_module(os.path.join(cell.root, "bench", "metrics",
                                       f"{m['name']}.py"),
                          "bench_metric_" + m["name"].replace(".", "_")
                          .replace("-", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def process_age() -> float:
    """Seconds since this process started (Linux)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)


def use_cache(root: str) -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, for the benchmark and the program alike, and cache every
    program so that only a checkout's first run compiles."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_pool(cfg: dict, traffic: dict, seeds, mesh=None
              ) -> tuple[list, list]:
    """Each episode seed's state and the keys of its chunks, committed to
    the state's device like every other input, or replicated over the
    plan's ``mesh`` (so that the chunk program is compiled for one
    signature only)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from bench.builders.system import make_state, seed_key

    chunk, ep = int(traffic["chunk_steps"]), int(traffic["episode_steps"])
    if ep % chunk:
        raise ValueError("episode_steps must be a multiple of chunk_steps")
    states, keys = [], []
    for seed in seeds:
        state = make_state(cfg, traffic, seed)
        k = jax.device_put(jax.random.split(
            jax.random.fold_in(seed_key(seed), 1), ep // chunk),
            state.pos.sharding if mesh is None
            else NamedSharding(mesh, PartitionSpec()))
        states.append(state)
        keys.append([k[c] for c in range(k.shape[0])])
    return states, keys


class Episodes:
    """The traffic of a coupled cell: a fixed pool of episodes, each a
    seeded state and its chunks' keys (:func:`make_pool`), each chunk one
    ``Engine.run`` call.  An episode restarts through the engine's own
    restart path (``state`` swapped, ``run(0)``: a table build and a force
    evaluation).  The window plays the pool in ``order``.  Every episode is
    the same sequence of work, whatever the speed of the program.

    ``carries`` is a stand-in, to be deleted once ``Engine.run`` restarts
    a swapped state on the ``Sharded`` plan, which it does not yet: there,
    each state's carry is built in set-up by an engine of its own
    (:func:`run_cell`) and an episode restarts by taking it up, so the
    window holds no restart build or force call on that plan."""

    def __init__(self, eng, states, keys, chunk_steps: int, order=None,
                 carries=None):
        self.eng, self.states, self.keys = eng, states, keys
        self.chunk_steps = chunk_steps
        self.order = list(range(len(states))) if order is None else order
        self.carries = carries

    def restart(self, m: int):
        with annotate("bench.restart"):
            if self.carries is not None:
                self.eng._carry = self.carries[m]
                return
            self.eng.state = self.states[m]
            self.eng.run(0, self.keys[m][0], chunk=self.chunk_steps)

    def chunk(self, m: int, c: int):
        with annotate("bench.chunk"):
            self.eng.run(self.chunk_steps, self.keys[m][c],
                         chunk=self.chunk_steps)

    def episode(self, m: int):
        """Play episode ``m``; returns the carry after its restart, each
        chunk's outputs (:func:`bench.check.chunk_outputs`), and how many
        chunks read non-finite."""
        import numpy as np

        from bench.check import chunk_outputs

        self.restart(m)
        c0, outs, bad = self.eng._carry, [], 0
        for c in range(len(self.keys[m])):
            self.chunk(m, c)
            outs.append(chunk_outputs(self.eng._carry))
            bad += int(not all(np.isfinite(v).all()
                               for v in self.eng.trace.values.values()))
        return c0, outs, bad

    def window(self, seconds: float) -> dict:
        """Replay the whole pool, in order, until ``seconds`` have passed;
        the window ends with the pass in which time ran out, on
        ``block_until_ready``, so that it holds whole passes only."""
        import jax

        eng = self.eng
        counts = []     # each episode's build counts, read after the window
        passes = nonfinite = 0
        built0 = eng.counters()["restart_builds"]
        t0 = time.perf_counter()
        with annotate(WINDOW):
            while True:
                for m in self.order:
                    c0, outs, bad = self.episode(m)
                    nonfinite += bad
                    counts.append((c0.n_rebuilds, outs[-1]["n_rebuilds"]))
                passes += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(eng._carry)
        elapsed = time.perf_counter() - t0
        restarts = passes * len(self.order)
        chunks = passes * sum(len(self.keys[m]) for m in self.order)
        return {"steps": chunks * self.chunk_steps, "restarts": restarts,
                "restart_builds": eng.counters()["restart_builds"] - built0,
                "chunks": chunks, "nonfinite_chunks": nonfinite,
                "window_s": elapsed,
                "rebuilds": sum(int(b) - int(a) for a, b in counts),
                "last": m, "carries": (c0, outs, eng._carry)}


def peak_memory(devices) -> int | None:
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        v = stats.get("peak_bytes_in_use")
        if v is not None:
            peak = max(peak or 0, int(v))
    return peak


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, *, require_accelerator: bool = True,
             started: float | None = None) -> tuple[int, dict | None]:
    """One run of ``workload``; returns (exit code, result line)."""
    t_start = started if started is not None else time.perf_counter()
    cell = load_cell(root, workload)

    import jax
    import numpy as np

    from bench import check
    from bench.builders import system
    from repro.telemetry.metrics import CompileWatchdog

    devices = jax.devices()
    platform = devices[0].platform
    chips = int(cell.workload["chips"])
    if require_accelerator and (platform == "cpu" or len(devices) < chips):
        print(f"bench: cell {workload} needs {chips} accelerator chip(s); "
              f"JAX finds {len(devices)} {platform} device(s)",
              file=sys.stderr)
        return UNSUPPORTED, None
    # the XLA:CPU executables of a test run are not worth keeping on disk
    cache = use_cache(root) if platform != "cpu" else None
    lim = check.limits(root, cell.config["name"])

    setup = {}
    wd = CompileWatchdog()
    mark = t_start

    def phase(name):
        nonlocal mark
        now = time.perf_counter()
        setup[name] = now - mark
        mark = now

    phase("imports")
    seeds = cell.traffic["episode_seeds"]
    order = [int(i) for i in np.random.default_rng(seed).permutation(
        len(seeds))]
    build = builder(cell)
    potential = build.make_potential(cell.config)
    jax.block_until_ready(getattr(potential, "params", None))
    phase("weights")
    mesh = system.make_mesh(cell.config)
    states, keys = make_pool(cell.config, cell.traffic, seeds, mesh)
    plan = system.make_plan(cell.config, mesh, states)
    phase("state")
    eng = system.make_engine(cell.config, cell.traffic, potential,
                             states[order[0]], plan)
    carries = None if mesh is None else [
        eng._carry if m == order[0] else system.make_engine(
            cell.config, cell.traffic, potential, s, plan)._carry
        for m, s in enumerate(states)]
    episodes = Episodes(eng, states, keys, cell.traffic["chunk_steps"], order,
                        carries)
    jax.block_until_ready((eng._carry, carries))
    phase("engine")
    m = order[0]
    episodes.restart(m)
    # the chunk program is compiled (or loaded) twice: the engine's rebuild
    # counter is an uncommitted array until the first chunk returns it
    for c in range(min(2, len(episodes.keys[m]))):
        episodes.chunk(m, c)
    jax.block_until_ready(eng._carry)
    phase("warm_chunk")
    episodes.restart(m)
    jax.block_until_ready(eng._carry)
    phase("warm_restart")
    setup_s = time.perf_counter() - t_start
    print("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in setup.items())
          + f"; total {setup_s:.3f} s; compiles "
          f"{wd.count} ({wd.seconds:.1f} s); cache {cache}", file=sys.stderr,
          flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    compiles0 = wd.count
    if traced:
        from bench import trace
        trace.start(trace_dir)
    try:
        rec = episodes.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    compiles = wd.count - compiles0
    peak = peak_memory(devices[:chips])
    kind = devices[0].device_kind
    n_atoms = int(episodes.states[0].pos.shape[0])
    rec.update(atoms=n_atoms, chips=chips, setup_s=setup_s,
               compiles=compiles, device_kind=kind, platform=platform,
               config=cell.config, traffic=cell.traffic,
               force_calls=rec["steps"] + rec["rebuilds"]
               + rec["restart_builds"])
    print(f"window: {rec['steps']} steps in {rec['chunks']} chunks, "
          f"{rec['restarts']} episode restarts, {rec['rebuilds']} in-scan "
          f"rebuilds, {rec['window_s']:.3f} s, {compiles} compiles",
          file=sys.stderr, flush=True)

    summary = None
    if traced:
        summary = trace.summarize(trace.load(trace_dir), chips=chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the comparison runs after the window and the memory reading, with
    # the program's state freed
    last = rec.pop("last")
    cap = check.capture(episodes.states[last], *rec.pop("carries"),
                        episodes.keys[last], episodes.chunk_steps,
                        cell.config.get("plan"))
    del episodes, eng, potential, carries
    weights = jax.device_get(build.make_weights(cell.config))
    t_ref = time.perf_counter()
    ref = check.outputs(cell, cap, jax.numpy.float32, weights)
    nums = check.numbers(cell, cap, ref,
                         check.program_outputs(cap, ref["compared"]))
    print(f"reference: {time.perf_counter() - t_ref:.1f} s; in-scan rebuilds "
          f"per chunk {cap.builds}, the reference's {ref['trips']}, compared "
          f"chunk {ref['compared']}", file=sys.stderr)
    correct = check.verdict(nums, lim) and rec["nonfinite_chunks"] == 0

    ctx = {"run": rec, "trace": summary, "root": root}
    result = {
        "correct": bool(correct),
        "attempted": rec["chunks"],
        "failed": rec["nonfinite_chunks"],
        "metrics": read_metrics(cell, ctx, traced),
        "device": {"platform": platform, "kind": kind, "count": len(devices),
                   "memory_peak_bytes": peak},
    }
    if traced:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["checks"] = {k: {"value": nums[k], "limit": lim[k]} for k in lim}
    for k in lim:
        ok = math.isfinite(nums[k]) and nums[k] <= lim[k]
        print(f"check {k} {nums[k]!r} limit {lim[k]!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    return 0, result
