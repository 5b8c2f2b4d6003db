"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy and
idle time, the device operations that took the most time, and the idle
gaps by what the host was doing.

The run brackets the traced rounds in a host annotation called
``WINDOW`` and each request in one called ``bench.<request>``
(``jax.profiler.TraceAnnotation``).  Busy time is the union of the
intervals in which an operation ran on a device (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane), clipped to the window and averaged over
the devices.  An operation is named by its program (the ``XLA Modules``
line) and its HLO name: ``jit__update_views_body/fusion.10``.  Each idle
stretch is put down to the innermost ``bench.*`` annotation open at its
midpoint.

    python3 -m bench.trace_reduce <trace.xplane.pb>
"""
from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.traced"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def _intervals(events) -> np.ndarray:
    """(n, 2) start/end nanoseconds, sorted by start."""
    iv = np.array([(s, s + d) for s, d, _ in events],
                  np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")]


def _union(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The union of the intervals, clipped to [lo, hi], as disjoint
    sorted intervals."""
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, np.float64).reshape(-1, 2)


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def op_name(text: str) -> str:
    """An operation's name from the HLO text the trace gives it
    (``%fusion.10 = u32[...] fusion(...)`` -> ``fusion.10``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _named_ops(lines) -> list:
    """The device's operations as (start, duration, name), each name
    prefixed with its program's (``jit_f/fusion.10``)."""
    lines = list(lines)
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(", 1)[0])
                  for ln in lines if ln.name == MODULES_LINE
                  for e in ln.events)
    starts = [m[0] for m in mods]
    out = []
    for ln in lines:
        if ln.name != OPS_LINE:
            continue
        for e in ln.events:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mods[k][2] + "/" if k >= 0 and mods[k][1] >= \
                e.start_ns else ""
            out.append((e.start_ns, e.duration_ns, mod + op_name(e.name)))
    return out


def reduce_planes(planes) -> Dict:
    """The reduction of an already-read trace (``ProfileData.planes``)."""
    window = None
    notes: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(_named_ops(plane.lines))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith("bench."):
                    notes.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len("bench."):]))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError(f"trace has no {DEVICE_PREFIX}* plane")
    lo, hi = window
    busy_ns, op_ns, gap_ns = [], {}, {}
    notes.sort()
    starts = [n[0] for n in notes]
    for events in devices:
        events = [e for e in events if e[0] < hi and e[0] + e[1] > lo]
        for start, dur, name in events:
            d = min(hi, start + dur) - max(lo, start)
            op_ns[name] = op_ns.get(name, 0.0) + d
        busy = _union(_intervals(events), lo, hi)
        busy_ns.append(float((busy[:, 1] - busy[:, 0]).sum()))
        for s, e in _gaps(busy, lo, hi):
            mid = (s + e) / 2
            what = "other"
            # innermost annotation open at the midpoint: the latest start
            for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                if notes[k][1] >= mid:
                    what = notes[k][2]
                    break
            gap_ns[what] = gap_ns.get(what, 0.0) + (e - s)
    n = len(devices)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy_ns) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": n,
            "device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v / n / 1e9] for k, v in gaps]}


def reduce_file(path) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
