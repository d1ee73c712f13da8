"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps and
top operations.

Device planes are those named ``/device:TPU:<n>``.  On each, the operations
are the events of its "XLA Ops" line, named by their HLO instruction; busy
time is the union of their intervals (nested or overlapping events count
once), and an idle gap is a stretch between two busy intervals.  Each gap is named by the host span of
the benchmark's own ``TraceAnnotation`` (``frame``, ``snapshot``, ``batch``,
``bucket``, ``merge``...) that covers the gap's midpoint, or ``other``.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# control-flow ops span the ops they run; they count for busy time (the
# union) but not among the top operations, whose time their children carry
CONTAINER = re.compile(r"^%(while|conditional|call)\b")


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` intervals into disjoint sorted ones."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.array(out)


def _host_spans(planes, names: set) -> list:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return sorted(spans)


UNIT_SPANS = ("frame", "batch")
SPAN_NAMES = ("frame", "snapshot", "batch", "bucket", "merge")


def reduce_planes(planes, window: tuple[float, float] | None = None) -> dict:
    """``planes``: the planes of a ``jax.profiler.ProfileData``.  ``window``
    (ns, on the trace's clock) clips the device intervals; default: from the
    start of the first to the end of the last unit span (``frame`` or
    ``batch``) on the host, else from the first to the last device operation."""
    planes = list(planes)  # ProfileData yields its planes once
    devices = {}
    ops: dict = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                name = ev.name.split(" = ")[0]  # the HLO instruction, without its text
                if not CONTAINER.match(name):
                    ops[name] = ops.get(name, 0.0) + ev.duration_ns
        devices[int(m.group(1))] = np.array(iv, dtype=np.float64).reshape(-1, 2)
    if not devices or not any(len(v) for v in devices.values()):
        return {}
    spans = _host_spans(planes, set(SPAN_NAMES))
    units = [(a, b) for a, b, n in spans if n in UNIT_SPANS]
    if window is None and units:
        window = (float(min(a for a, _ in units)), float(max(b for _, b in units)))
    if window is None:
        allv = np.concatenate([v for v in devices.values() if len(v)])
        window = (float(allv[:, 0].min()), float(allv[:, 1].max()))
    w0, w1 = window
    busy, gaps = {}, []
    for dev, iv in sorted(devices.items()):
        u = _union(np.clip(iv, w0, w1))
        u = u[u[:, 1] > u[:, 0]]
        busy[dev] = float((u[:, 1] - u[:, 0]).sum()) * 1e-9
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                mid = 0.5 * (s + e)
                # the innermost (latest-starting) host span covering the gap
                name = next((n for a, b, n in reversed(spans) if a <= mid < b), "other")
                gaps.append((name, (e - s) * 1e-9, dev))
    window_s = (w1 - w0) * 1e-9
    by_name: dict = {}
    for name, sec, _ in gaps:
        by_name[name] = by_name.get(name, 0.0) + sec
    n_dev = len(busy)
    return dict(
        window_s=window_s,
        busy_s=busy,
        busy_s_mean=sum(busy.values()) / n_dev,
        idle_share=max(1.0 - b / window_s for b in busy.values()) if window_s > 0 else None,
        device_ops=sorted(((k, v * 1e-9 / n_dev) for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:10],
        idle_gaps=sorted(((k, v / n_dev) for k, v in by_name.items()), key=lambda kv: -kv[1])[:10],
    )


def find_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_file(path: str, window=None) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window)
