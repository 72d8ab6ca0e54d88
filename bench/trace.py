"""Reduction of a profiler trace to per-layer device time.

:func:`start` traces with the Python function tracer off (host annotations
and runtime events stay), so that tracing slows the host little;
:func:`load` reads the ``*.trace.json.gz`` that ``jax.profiler`` writes
beside the ``.xplane.pb`` into a plain event list: each chip's ops from
its ``XLA Ops`` line, with the named-scope path XLA keeps for the op
(``tf_op``), and the host's spans.  On XLA:CPU, where ops run on host
threads, those ops stand in for one device (for the tests).
:func:`summarize` reduces that list, inside the host span ``bench.window``,
to

* ``busy_s``: the union of the intervals in which an op ran on a chip,
  averaged over the chips, and ``window_s``;
* ``scopes``: device seconds per step phase, each op's own time (less the
  ops nested in it) counted under the innermost ``repro.<phase>`` named
  scope it carries (``force``,
  ``rebuild``, ``integrate``, ``observe``, ``halo``; ``other`` without one);
* ``breakdown``: the ten device ops that took most time, and the idle gaps
  summed by the innermost host span that covers each gap's middle (gaps
  under 10 us, between back-to-back ops, are summed under one name).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

import numpy as np

WINDOW = "bench.window"   # the harness's host span around the window
SMALL_GAP_NS = 10_000    # gaps shorter than this are summed, not attributed
SMALL_GAP = "gaps under 10 us"
_SCOPE = re.compile(r"repro\.([A-Za-z_]+)")


def _scope(tf_op: str) -> str:
    """Innermost ``repro.<phase>`` scope of an op's scope path."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else "other"


def start(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def load(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir``:
    ``{"devices": {name: [[start_ns, dur_ns, op, scope], ...]},
    "host": [[start_ns, dur_ns, name], ...]}``."""
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
        if proc.startswith("/device:") and "CPU" not in proc:
            if threads.get((e["pid"], e["tid"])) == "XLA Ops":
                devices.setdefault(proc, []).append(
                    [start_ns, dur_ns, e["name"], _scope(args.get("tf_op"))])
        elif proc.startswith("/host:") and dur_ns > 0:
            if "hlo_op" in args:      # XLA:CPU runs ops on host threads
                cpu_ops.append([start_ns, dur_ns, e["name"],
                                _scope(args.get("tf_op"))])
            else:
                host.append([start_ns, dur_ns, e["name"]])
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "host": host}


def _self_time(ops) -> list:
    """Each op's duration less the ops nested inside it (a ``while`` or
    ``conditional`` op spans the ops of its body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [op[1] for op in ops]
    stack = []
    for i in order:
        s, e = ops[i][0], ops[i][0] + ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][0] + ops[stack[-1]][1]:
            own[stack[-1]] -= ops[i][1]
        stack.append(i)
    return own


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _top(totals: dict, n: int = 10):
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summarize(events: dict, chips: int = 1) -> dict:
    """Busy time, per-scope device time and the breakdown inside the
    ``bench.window`` host span (the whole trace where it is missing)."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device ops")
    spans = [(s, s + d) for s, d, name in events["host"] if name == WINDOW]
    if spans:
        w0, w1 = spans[-1]
    else:
        w0 = min(op[0] for ops in devices.values() for op in ops)
        w1 = max(op[0] + op[1] for ops in devices.values() for op in ops)
    busy, scopes, ops_time, gaps = 0.0, {}, {}, []
    planes = sorted(devices)[:chips]
    for plane in planes:
        clipped = []
        for (s, d, name, scope), own in zip(devices[plane],
                                            _self_time(devices[plane])):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            sec = own * (b - a) / d * 1e-9 if d > 0 else 0.0
            scopes[scope] = scopes.get(scope, 0.0) + sec
            key = f"repro.{scope}:{name}" if scope != "other" else name
            ops_time[key] = ops_time.get(key, 0.0) + sec
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(planes)
    hs = np.array([s for s, d, name in events["host"] if name != WINDOW])
    he = hs + np.array([d for s, d, name in events["host"] if name != WINDOW])
    names = [name for s, d, name in events["host"] if name != WINDOW]
    idle = {}
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            name = SMALL_GAP
        else:
            mid = 0.5 * (a + b)
            cover = np.nonzero((hs <= mid) & (he >= mid))[0]
            name = (names[cover[np.argmin(he[cover] - hs[cover])]]
                    if cover.size else "no host span")
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9 / n
    return {"busy_s": busy / n, "window_s": (w1 - w0) * 1e-9,
            "scopes": {k: v / n for k, v in scopes.items()},
            "breakdown": {"device_ops": _top({k: v / n
                                              for k, v in ops_time.items()}),
                          "idle_gaps": _top(idle)}}
