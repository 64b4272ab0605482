"""From a profiler trace to the device's busy time, its top operations and
its idle gaps, each gap put down to what the host was doing in it.

An event is ``(plane, line, name, start_ns, duration_ns)``.  Device time
is read from the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host
activity from the harness's own spans (``jax.profiler.TraceAnnotation``)
on the ``/host:CPU`` plane, which shares the trace's clock.  The traced
window is the harness's ``traced_window`` span.

Each iteration of a device loop is an op of its own, so the ops of a
request run to hundreds of thousands.  Where the profiler's buffers
fill, it drops the rest and marks the place with a ``Trace Buffers
Dropped`` event on the device plane; the window then ends with the last
request that ended before it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "traced_window"
REQUEST_SPAN = "request"
DROPPED = "Trace Buffers Dropped"
TOP = 10


def start(trace_dir: str) -> None:
    """Start the profiler with its Python function tracer off: it records
    every Python call of the host, which slows the run and fills the
    trace; the harness's spans are recorded without it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def read_xplane(trace_dir: str, host_spans) -> list:
    """The events of the one ``.xplane.pb`` under ``trace_dir`` that the
    reduction reads: device ops, the device's marks of dropped buffers,
    and host spans named in ``host_spans``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    wanted = set(host_spans) | {WINDOW_SPAN, REQUEST_SPAN}
    out = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.extend((plane.name, line.name, ev.name, ev.start_ns,
                                ev.duration_ns) for ev in line.events)
                else:
                    out.extend((plane.name, line.name, ev.name, ev.start_ns,
                                ev.duration_ns)
                               for ev in line.events if ev.name == DROPPED)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out.extend((plane.name, line.name, ev.name, ev.start_ns,
                            ev.duration_ns)
                           for ev in line.events if ev.name in wanted)
    return out


def merge(intervals) -> np.ndarray:
    """Union of ``(start, end)`` intervals as sorted, disjoint rows."""
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > ends[:-1]])
    last = np.r_[first[1:] - 1, len(iv) - 1]
    return np.stack([iv[first, 0], ends[last]], axis=1)


def reduce(events) -> dict:
    """Busy and idle time of the devices inside the traced window.

    Returns ``busy_s`` (union of op intervals, mean over device planes),
    ``window_s``, ``requests`` (request spans wholly inside the window),
    ``device_ops`` (top ops by summed seconds, all planes), ``idle_gaps``
    (idle seconds by the innermost host span over each part of a gap,
    ``between requests`` where only the window covers it) and
    ``op_seconds``, the summed seconds of every op name, and ``dropped``,
    whether the window was cut where the profiler dropped its buffers."""
    host = [e for e in events if e[0] == HOST_PLANE]
    win = [e for e in host if e[2] == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(win)}")
    w0, w1 = win[0][3], win[0][3] + win[0][4]
    drops = [e[3] for e in events
             if e[0].startswith(DEVICE_PREFIX) and e[2] == DROPPED
             and e[3] < w1]
    if drops:
        cut = min(drops)
        ends = [s + d for p, _, n, s, d in host
                if n == REQUEST_SPAN and s >= w0 and s + d <= cut]
        if not ends:
            raise ValueError("the profiler dropped its buffers before the "
                             "first traced request ended")
        w1 = max(ends)
    planes = defaultdict(list)
    op_s = defaultdict(float)
    for plane, _, name, start, dur in events:
        if not plane.startswith(DEVICE_PREFIX) or name == DROPPED:
            continue
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            planes[plane].append((s, e))
            op_s[name] += (e - s) * 1e-9
    if not planes:
        raise ValueError("no device operation ran inside the traced window")
    busy = [merge(iv) for iv in planes.values()]
    busy_s = float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in busy])) * 1e-9
    spans = [(s, s + d, n) for p, _, n, s, d in host
             if n != WINDOW_SPAN and s < w1 and s + d > w0]
    gaps = defaultdict(float)
    allbusy = merge(np.concatenate(busy))
    edges = np.concatenate([[w0], allbusy.ravel(), [w1]]).reshape(-1, 2)
    if len(spans):
        ss = np.asarray([s for s, _, _ in spans], dtype=np.float64)
        se = np.asarray([e for _, e, _ in spans], dtype=np.float64)
        sn = [n for _, _, n in spans]
    for g0, g1 in edges:
        if g1 <= g0:
            continue
        if not len(spans):
            gaps["between requests"] += (g1 - g0) * 1e-9
            continue
        # split the gap where a host span starts or ends inside it, and put
        # each piece down to the innermost span that covers it
        near = np.flatnonzero((ss < g1) & (se > g0))
        cuts = np.unique(np.clip(np.r_[g0, g1, ss[near], se[near]], g0, g1))
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = (a + b) / 2
            cover = near[(ss[near] <= mid) & (se[near] >= mid)]
            label = (sn[cover[np.argmin(se[cover] - ss[cover])]]
                     if len(cover) else "between requests")
            gaps[label] += (b - a) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]

    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) * 1e-9,
        "requests": sum(1 for s, e, n in spans
                        if n == REQUEST_SPAN and s >= w0 and e <= w1),
        "device_ops": top(op_s),
        "idle_gaps": top(gaps),
        "op_seconds": dict(op_s),
        "dropped": bool(drops),
    }
