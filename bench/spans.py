"""Sub-phases, host spans and program counters of a traced cell run.

:mod:`bench.trace` reduces each device op to its innermost
``repro.<phase>`` scope and is left as it is.  The program also names
dotted sub-phases beneath its phases (``repro.rebuild.search``,
``repro.force.adjoint``, ...), marks its host work with spans
(``repro.run``, ``repro.chunk`` and its parts ``.lower`` / ``.enqueue`` /
``.wait`` / ``.gate``, ``repro.restart``, ``repro.sync``) and counts its
own work (``Engine.counters()``).  This module reads those:

* :func:`load` returns :func:`bench.trace.load`'s events with a fifth field
  on each device op, the dotted sub-scopes of its ``tf_op`` path
  (``["rebuild.search"]``); :func:`strip` drops it again;
* :func:`summarize` reduces them inside ``bench.window`` to ``parts``
  (device seconds per sub-scope, each op's own time counted under every
  sub-scope of its path), ``covered`` (per phase, the device seconds of
  ops that carry a sub-scope of it; ``force.after_build`` names the
  rebuild's force call and does not count), ``unscoped_s`` (under no
  ``repro.*`` scope), ``host_spans`` (per ``repro.*`` span: count, seconds,
  seconds not under a ``repro.chunk.wait``) and ``idle`` (each idle gap
  under the innermost ``repro.*`` or ``bench.*`` host span covering its
  middle);
* :data:`METRICS` computes five per-layer numbers from both reductions,
  the run record and the counters' deltas over the window.

Run one traced window of a cell, as ``bench/run.py --trace 1`` does, and
print these on the last line of standard output after the result line::

    python3 bench/spans.py --workload nep-fc-64k --seed 7 --seconds 30

``--small`` runs the cell at the 512-atom size of the benchmark's own
tests; ``--events PATH`` keeps the loaded events (gzipped JSON, with the
window's counters).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

import numpy as np

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402

WAIT = "repro.chunk.wait"   # where the host blocks on the device
AFTER_BUILD = "force.after_build"
_PART = re.compile(r"repro\.([A-Za-z_]+(?:\.[A-Za-z_]+)+)")


def parts_of(tf_op: str) -> list:
    """Dotted ``repro.<phase>.<part>`` sub-scopes of an op's scope path,
    outermost first, each once."""
    return list(dict.fromkeys(_PART.findall(tf_op or "")))


def load(trace_dir: str) -> dict:
    """:func:`bench.trace.load`'s events of the newest trace under
    ``trace_dir``, each device op as ``[start_ns, dur_ns, op, scope,
    parts]``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    with gzip.open(paths[-1], "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices, host, cpu_ops = {}, [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        start_ns, dur_ns = 1e3 * float(e["ts"]), 1e3 * float(e.get("dur", 0))
        args = e.get("args") or {}
        tf_op = args.get("tf_op")
        op = [start_ns, dur_ns, e["name"], trace._scope(tf_op),
              parts_of(tf_op)]
        if proc.startswith("/device:") and "CPU" not in proc:
            if threads.get((e["pid"], e["tid"])) == "XLA Ops":
                devices.setdefault(proc, []).append(op)
        elif proc.startswith("/host:") and dur_ns > 0:
            if "hlo_op" in args:      # XLA:CPU runs ops on host threads
                cpu_ops.append(op)
            else:
                host.append([start_ns, dur_ns, e["name"]])
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "host": host}


def strip(events: dict) -> dict:
    """The events as :func:`bench.trace.load` gives them (four fields)."""
    return {"devices": {k: [op[:4] for op in ops]
                        for k, ops in events["devices"].items()},
            "host": events["host"]}


def _window(events: dict) -> tuple:
    spans = [(s, s + d) for s, d, name in events["host"]
             if name == trace.WINDOW]
    if spans:
        return spans[-1]
    ops = [op for ops in events["devices"].values() for op in ops]
    return (min(op[0] for op in ops), max(op[0] + op[1] for op in ops))


def _overlap(a: float, b: float, intervals) -> float:
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in intervals)


def summarize(events: dict, chips: int = 1) -> dict:
    """Sub-scope device time, host spans and idle attribution inside
    ``bench.window``; events with four fields per op give no parts."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device ops")
    w0, w1 = _window(events)
    planes = sorted(devices)[:chips]
    parts, covered, unscoped, gaps = {}, {}, 0.0, []
    for plane in planes:
        ops, clipped = devices[plane], []
        for op, own in zip(ops, trace._self_time(ops)):
            s, d, scope = op[0], op[1], op[3]
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            sec = own * (b - a) / d * 1e-9 if d > 0 else 0.0
            names = op[4] if len(op) > 4 else []
            for part in names:
                parts[part] = parts.get(part, 0.0) + sec
            if any(p.startswith(scope + ".") and p != AFTER_BUILD
                   for p in names):
                covered[scope] = covered.get(scope, 0.0) + sec
            unscoped += sec if scope == "other" else 0.0
        merged = trace._union(clipped)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(planes)
    spans = [(max(s, w0), min(s + d, w1), name)
             for s, d, name in events["host"]
             if name.startswith(("repro.", "bench.")) and name != trace.WINDOW
             and min(s + d, w1) > max(s, w0)]
    waits = [(a, b) for a, b, name in spans if name == WAIT]
    host_spans = {}
    for a, b, name in spans:
        if not name.startswith("repro."):
            continue
        h = host_spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                         "unwaited_s": 0.0})
        h["count"] += 1
        h["seconds"] += (b - a) * 1e-9
        h["unwaited_s"] += (b - a - _overlap(a, b, waits)) * 1e-9
    hs = np.array([a for a, _, _ in spans])
    he = np.array([b for _, b, _ in spans])
    idle = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = np.nonzero((hs <= mid) & (he >= mid))[0]
        name = (spans[cover[np.argmin(he[cover] - hs[cover])]][2]
                if cover.size else trace.WINDOW)
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9 / n
    idle_s = sum(idle.values())
    return {"parts": {k: v / n for k, v in parts.items()},
            "covered": {k: v / n for k, v in covered.items()},
            "unscoped_s": unscoped / n,
            "host_spans": host_spans,
            "idle": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "idle_in_repro": (sum(v for k, v in idle.items()
                                  if k.startswith("repro.")) / idle_s
                              if idle_s > 0 else None)}


# ---------------------------------------------------------------------------
# per-layer metrics, each a device or host time over a program count of the
# window; a reader takes ctx = {"run": {"counters": Engine.counters()
# deltas over the window}, "trace": bench.trace's summary, "spans": this
# module's} and gives None where there is nothing to read
# ---------------------------------------------------------------------------

def _ms_per(seconds, count):
    def read(ctx):
        t, sp = ctx.get("trace"), ctx.get("spans")
        c = (ctx.get("run") or {}).get("counters")
        if not (t and sp and c):
            return None
        x, n = seconds(t, sp), count(c)
        return 1e3 * x / n if x is not None and n else None
    return read


def _builds(c):
    return c["rebuilds"] + c["restart_builds"]


METRICS = {
    # repro.rebuild device time per table build, in-scan and at restarts
    "rebuild_ms_per_build": _ms_per(
        lambda t, sp: t["scopes"].get("rebuild"), _builds),
    # the stencil search: candidate gathers, distance test, top_k
    "cell_search_ms_per_build": _ms_per(
        lambda t, sp: sp["parts"].get("rebuild.search"), _builds),
    # the NEP kernels' neighbour-adjoint gather, per force call
    "nep_adjoint_ms_per_call": _ms_per(
        lambda t, sp: sp["parts"].get("force.adjoint"),
        lambda c: c["force_calls"]),
    # the autodiff force's pair scatter, per force call
    "pair_scatter_ms_per_call": _ms_per(
        lambda t, sp: sp["parts"].get("force.assemble"),
        lambda c: c["force_calls"]),
    # host time inside repro.run not under repro.chunk.wait, per chunk
    "host_ms_per_chunk": _ms_per(
        lambda t, sp: sp["host_spans"].get("repro.run", {}).get(
            "unwaited_s"),
        lambda c: c["chunks"]),
}


def read_metrics(ctx) -> dict:
    out = {name: read(ctx) for name, read in METRICS.items()}
    return {k: v for k, v in out.items() if v is not None}


# ---------------------------------------------------------------------------
# one traced run of a cell
# ---------------------------------------------------------------------------

def _counted(episodes_cls, captured: dict):
    """``Episodes`` whose window also records the engine's counters' deltas
    (read before the clock starts and after it stops); a program without
    ``Engine.counters`` records none."""

    class Counted(episodes_cls):
        def window(self, seconds):
            read = getattr(self.eng, "counters", None)
            c0 = read() if read else None
            rec = super().window(seconds)
            if c0 is not None:
                c1 = read()
                captured["counters"] = {k: c1[k] - c0[k] for k in c1}
            return rec

    return Counted


def _small(load_cell):
    """``load_cell`` at the size of the benchmark's own tests."""
    from bench.tests.conftest import SHORT, SMALL

    def small(root, workload):
        cell = load_cell(root, workload)
        cell.config.update(SMALL)
        cell.traffic.update(SHORT)
        return cell

    return small


def run(root: str, workload: str, seed: int, seconds: float, *,
        small: bool = False, require_accelerator: bool = True):
    """One traced run of ``workload`` through :func:`bench.harness.run_cell`
    with this module's reductions beside the harness's own.  The harness is
    left as it is: for this one call its trace loading also keeps the
    events with their sub-scopes (before the harness deletes the trace),
    and its window also reads the counters.  Returns (exit code, result
    line, this module's summary or None, events or None)."""
    from bench import harness

    captured = {}
    saved = trace.load, harness.Episodes, harness.load_cell

    def load_both(trace_dir):
        captured["events"] = load(trace_dir)
        return saved[0](trace_dir)

    trace.load = load_both
    harness.Episodes = _counted(saved[1], captured)
    if small:
        harness.load_cell = _small(saved[2])
    try:
        chips = int(harness.load_cell(root, workload).workload["chips"])
        rc, result = harness.run_cell(root, workload, seed, seconds, True,
                                      require_accelerator=require_accelerator)
    finally:
        trace.load, harness.Episodes, harness.load_cell = saved
    if result is None:
        return rc, None, None, None
    events = captured["events"]
    sp = summarize(events, chips=chips)
    t = trace.summarize(strip(events), chips=chips)
    ctx = {"run": {"counters": captured.get("counters")}, "trace": t,
           "spans": sp}
    out = {"metrics": read_metrics(ctx), "counters": ctx["run"]["counters"],
           "scopes": t["scopes"], "busy_s": t["busy_s"],
           "window_s": t["window_s"], **sp}
    return rc, result, out, {**events, "counters": out["counters"]}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--events")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc, result, out, events = run(root, args.workload, args.seed,
                                  args.seconds, small=args.small)
    if result is None:
        return rc
    print(json.dumps(result), flush=True)
    print("idle by span: " + ", ".join(f"{k} {v * 1e3:.3f} ms"
                                       for k, v in out["idle"].items()),
          file=sys.stderr)
    if args.events:
        with gzip.open(args.events, "wt") as f:
            json.dump(events, f)
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
