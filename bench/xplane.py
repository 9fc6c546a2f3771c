"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Reading is split from reducing, so the reduction can be checked on a
small recorded trace and on hand-made event lists alike:

* :func:`read_planes` turns an ``.xplane.pb`` into plain tuples
  ``(plane, [(line, [(name, start_ns, dur_ns), ...]), ...])`` with
  ``jax.profiler.ProfileData`` (it imports JAX only when called);
* :func:`reduce` takes those tuples and the name of the host span that
  marks the measured window, and returns the device's busy time (the
  union of the intervals in which an operation ran, averaged over the
  device planes), the window's length, the share idle, device time per
  operation and per module, and the longest idle gaps, each labelled with
  the innermost benchmark host span open at its middle.

Device planes are those named ``/device:<KIND>:<n>``; their operations
are the ``XLA Ops`` line, their compiled programs the ``XLA Modules``
line.  Host spans are the events on ``/host:`` planes whose names start
with ``bench.``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."

Event = Tuple[str, float, float]
Plane = Tuple[str, List[Tuple[str, List[Event]]]]


def find_xplane(directory: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_planes(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(ev.name, float(ev.start_ns),
                                       float(ev.duration_ns))
                                      for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(planes: List[Plane], window_span: str, top: int = 10) -> Dict:
    """Busy union, idle share, per-op and per-module device time, and the
    longest idle gaps inside the host span named ``window_span``."""
    host_spans: List[Event] = []
    device = []
    for name, lines in planes:
        if name.startswith("/host:"):
            for _line, events in lines:
                host_spans.extend(e for e in events
                                  if e[0].startswith(SPAN_PREFIX))
        elif _DEVICE_PLANE.match(name):
            device.append(dict(lines))
    windows = [e for e in host_spans if e[0] == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0 = min(e[1] for e in windows)
    w1 = max(e[1] + e[2] for e in windows)
    window_ns = w1 - w0

    busy_ns = []
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    all_busy: List[Tuple[float, float]] = []
    for lines in device:
        events = lines.get(OPS_LINE)
        if events is None:
            events = [e for evs in lines.values() for e in evs]
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _merge(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        all_busy.extend(merged)
        for name, s, d in lines.get(MODULES_LINE, []):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                modules[name] = modules.get(name, 0.0) + (b - a) * 1e-9
    n_dev = max(len(device), 1)
    busy_s = sum(busy_ns) / n_dev * 1e-9

    # Idle gaps of the union over devices, labelled by the innermost
    # benchmark span open at the gap's middle.
    merged = _merge(all_busy)
    gaps = []
    cursor = w0
    for a, b in merged + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    inner = [e for e in host_spans if e[0] != window_span]
    labelled = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [e for e in inner if e[1] <= mid <= e[1] + e[2]]
        label = (min(open_, key=lambda e: e[2])[0] if open_
                 else window_span)
        labelled.append((label, (b - a) * 1e-9))
    labelled.sort(key=lambda g: -g[1])

    return {
        "devices": len(device),
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "idle_share": (1.0 - busy_s / (window_ns * 1e-9)) if window_ns else None,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "modules": modules,
        "idle_gaps": labelled[:top],
    }
