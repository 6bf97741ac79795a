"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* The window is the harness's ``chipbench.window`` annotation, on the
  line of the host plane that holds it (the Python thread's).
* Device operations are the events of each TPU plane's ``XLA Ops`` line
  (XLA fusions and Mosaic kernels alike), clipped to the window.  An
  event is named by its HLO instruction without the numeric suffix: a
  Mosaic kernel by its ``pallas_call`` name (``ring_conv_dw_q``), an XLA
  op by its kind (``convert_element_type``, ``copy_bitcast_fusion``).
* The device's clock runs apart from the host's (by about 1.5 ms on a
  v5e).  Device times are moved onto the host's clock by the smallest
  shift under which no program starts on the device before the host
  launched it: the k-th ``XLA Modules`` event is matched with the k-th
  ``tpu::System::Execute`` on the host.
* ``busy_s`` is the length of the union of those intervals, averaged
  over the devices that ran anything; ``window_s`` is the window.
* ``op_s`` sums each operation's time by name, over every device;
  ``device_ops`` is its top ``top``, longest first.  ``idle_gaps`` sums
  the device's idle time (on the first busy device) by what the host
  was doing at the middle of each gap: the innermost event of that
  Python line around it (a harness annotation, a jitted call's
  dispatch, a wait on a buffer), or ``"no host event"``.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

WINDOW = "chipbench.window"
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX, OPS_LINE = "/device:TPU:", "XLA Ops"
MODULES_LINE, LAUNCH = "XLA Modules", "tpu::System::Execute"


def find_xplane(root) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {root}, "
                                f"found {len(found)}")
    return found[0]


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_events(plane):
    """``(start, end, name)`` of the host line that holds the window."""
    for line in plane.lines:
        evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        if any(name == WINDOW for _, _, name in evs):
            return evs
    return []


def clock_shift(host_plane, device_plane) -> float:
    """Nanoseconds to add to the device's times to put them on the
    host's clock (0 when launches and programs cannot be matched)."""
    launches = sorted(e.start_ns for ln in host_plane.lines
                      for e in ln.events if e.name == LAUNCH)
    programs = sorted(e.start_ns for ln in device_plane.lines
                      if ln.name == MODULES_LINE for e in ln.events)
    if not programs or len(launches) != len(programs):
        return 0.0
    return max(h - d for h, d in zip(launches, programs))


def op_name(event_name: str) -> str:
    """``%ring_conv_dw_q.1 = s32[...] custom-call(...)`` -> ``ring_conv_dw_q``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def _innermost(host, points):
    """For each time in ``points``, the name of the innermost host event
    around it (events of one thread nest, so a stack sweep finds it)."""
    evs = sorted(host, key=lambda ev: (ev[0], -ev[1]))
    order = sorted(range(len(points)), key=points.__getitem__)
    names = ["no host event"] * len(points)
    stack: list = []
    i = 0
    for k in order:
        t = points[k]
        while i < len(evs) and evs[i][0] <= t:
            while stack and stack[-1][1] < evs[i][0]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if stack:
            names[k] = stack[-1][2]
    return names


def reduce(path, *, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_plane = data.find_plane_with_name(HOST_PLANE)
    host = _host_events(host_plane)
    wins = [(s, e) for s, e, name in host if name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found "
                         f"{len(wins)}")
    w0, w1 = wins[0]
    busy, per_op, first = [], defaultdict(float), None
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ivs = []
        shift = clock_shift(host_plane, plane)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns + shift, w0)
                e = min(ev.end_ns + shift, w1)
                if e > s:
                    ivs.append((s, e))
                    per_op[op_name(ev.name)] += (e - s) * 1e-9
        if ivs:
            merged = _union(ivs)
            busy.append(sum(e - s for s, e in merged))
            first = first or merged
    if not busy:
        raise ValueError("no device operation ran inside the window")
    edges = [w0] + [t for iv in first for t in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps = defaultdict(float)
    for (s, e), name in zip(idle, _innermost(host, [(s + e) / 2
                                                    for s, e in idle])):
        gaps[name] += (e - s) * 1e-9
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    ranked_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "devices": len(busy),
            "op_s": dict(per_op),
            "device_ops": [[n, s] for n, s in ranked],
            "idle_gaps": [[n, s] for n, s in ranked_gaps]}
