#!/usr/bin/env python3
"""Reduce the program's ``vmcu.*`` spans with the device trace.

The program's spans (``repro.obs.spans``, DESIGN.md §12) land on the
profiler's host line while it records, on the clock ``trace.py`` moves
the device's ops onto.  :func:`reduce` reads the same ``.xplane.pb`` as
``trace.reduce`` and gives, for the ``chipbench.window``:

* ``spans``: for each ``vmcu.*`` name on the window's host line, clipped
  to the window, ``count``, ``total_s`` and ``self_s`` (total less the
  ``vmcu.*`` spans directly inside it);
* ``span_idle``: the device's idle seconds (the gaps ``idle_gaps``
  names), split at the spans' edges, each piece to the innermost
  ``vmcu.*`` span around it.  Idle time under ``vmcu.sync`` (the host
  waiting on a device array) counts toward the span it waits in; idle
  time under no ``vmcu.*`` span counts as ``"outside"``.  The values sum
  to the window's idle time.  (``idle_gaps`` gives a whole gap to the
  event around its middle; a gap that runs from one call's last kernel
  into the next call's first would then go to one span alone.)

``run.reduce_trace`` merges both into the trace a run's readers get.
The readers ``layer_metrics/host_io_ms.py``, ``dispatch_ms.py``,
``host_io_wait.py`` and ``dispatch_wait.py`` take ``trace["spans"]`` and
``trace["span_idle"]``; they return ``None`` where a trace has no span.

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s>

sets the cell up as ``run.py`` does, measures ``--seconds`` with the
profiler off, then ``run.TRACE_SECONDS`` with it on, and prints one JSON
line: both windows' calls per second, spans per call, ``spans``,
``span_idle``, the idle share and the four readers' values.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parent / "src", HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import trace as tr  # noqa: E402

PREFIX, SYNC, OUTSIDE = "vmcu.", "vmcu.sync", "outside"
READERS = ("host_io_ms.eval", "dispatch_ms.eval", "host_io_wait.eval",
           "dispatch_wait.eval")


def _idle(data, host_plane, w0: float, w1: float) -> list:
    """``[(start, end)]`` in which the first device that ran anything
    inside the window ran nothing (``trace.reduce``'s gaps)."""
    for plane in data.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        shift = tr.clock_shift(host_plane, plane)
        ivs = [(max(ev.start_ns + shift, w0), min(ev.end_ns + shift, w1))
               for line in plane.lines if line.name == tr.OPS_LINE
               for ev in line.events]
        busy = tr._union([(s, e) for s, e in ivs if e > s])
        if busy:
            edges = [w0] + [t for iv in busy for t in iv] + [w1]
            return [(s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s]
    raise ValueError("no device operation ran inside the window")


def _nest(evs: list, t0: float, t1: float) -> tuple[list, list]:
    """For events inside ``[t0, t1]`` that nest (one thread's), each
    one's innermost enclosing event (``None`` at the top), and the
    ``[(start, end, i)]`` pieces of ``[t0, t1]`` with the innermost event
    ``i`` open over each (``None`` where none is)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    parent: list = [None] * len(evs)
    pieces: list = []
    stack: list = []
    t = t0

    def close(limit):
        nonlocal t
        while stack and evs[stack[-1]][1] <= limit:
            j = stack.pop()
            pieces.append((t, evs[j][1], j))
            t = evs[j][1]

    for i in order:
        close(evs[i][0])
        pieces.append((t, evs[i][0], stack[-1] if stack else None))
        t = evs[i][0]
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    close(t1)
    pieces.append((t, t1, None))
    return parent, [p for p in pieces if p[1] > p[0]]


def _overlaps(gaps: list, pieces: list):
    """``(seconds, i)`` of each overlap of the sorted, disjoint ``gaps``
    with the sorted, disjoint ``pieces``."""
    k = 0
    for s, e in gaps:
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < e:
            lo, hi = max(s, pieces[j][0]), min(e, pieces[j][1])
            if hi > lo:
                yield (hi - lo) * 1e-9, pieces[j][2]
            j += 1


def reduce(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_plane = data.find_plane_with_name(tr.HOST_PLANE)
    host = tr._host_events(host_plane)
    w0, w1 = next((s, e) for s, e, name in host if name == tr.WINDOW)
    evs = [(max(s, w0), min(e, w1), name) for s, e, name in host
           if name.startswith(PREFIX) and min(e, w1) > max(s, w0)]
    parent, pieces = _nest(evs, w0, w1)
    spans: dict = defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0})
    for (s, e, name), up in zip(evs, parent):
        spans[name]["count"] += 1
        spans[name]["total_s"] += (e - s) * 1e-9
        spans[name]["self_s"] += (e - s) * 1e-9
        if up is not None:
            spans[evs[up][2]]["self_s"] -= (e - s) * 1e-9
    idle: dict = defaultdict(float)
    for sec, i in _overlaps(_idle(data, host_plane, w0, w1), pieces):
        if i is not None and evs[i][2] == SYNC and parent[i] is not None:
            i = parent[i]
        idle[OUTSIDE if i is None else evs[i][2]] += sec
    return {"spans": dict(spans), "span_idle": dict(idle)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import run
    from repro.compile.cache import use_compile_cache

    bench = run.Bench()
    wl = bench.cell(args.workload)
    cfg = bench.config(wl["config"])
    run.device_check(wl["chips"])
    use_compile_cache()
    layers = bench.layers(cfg)
    cell = bench.mode(wl["mode"]).Cell(cfg, wl, args.seed, layers)
    cell.warm()
    off = run.measure(cell, args.seconds)
    with tempfile.TemporaryDirectory(prefix="chipbench-spans-") as tmp:
        rec = run.measure(cell, run.TRACE_SECONDS, tmp)
        path = tr.find_xplane(tmp)
        trace = run.reduce_trace(path)
    calls = rec["traced"]["calls"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "calls_per_s_off": off["calls"] / off["elapsed_s"],
        "calls_per_s_traced": calls / rec["traced"]["elapsed_s"],
        "traced_calls": calls,
        "spans_per_call": sum(v["count"] for v in trace["spans"].values())
        / calls,
        "window_s": trace["window_s"], "busy_s": trace["busy_s"],
        "idle_share": bench.reader("idle_share.eval", True).read(rec, trace),
        "spans": trace["spans"], "span_idle": trace["span_idle"],
        "idle_gaps": trace["idle_gaps"],
        "metrics": {m: bench.reader(m, True).read(rec, trace)
                    for m in READERS},
    }
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
