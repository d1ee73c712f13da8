"""Device time by the program's named scopes in a profiler trace
(``.xplane.pb``), and device idle time inside the program's host spans.

    python3 benchmarks/chip/scopes.py <trace.xplane.pb>   # prints the split

The program names the phases of its round loop with ``jax.named_scope``
(``repro.core.engine.PHASES``) and each subsystem hook with the subsystem's
name.  XLA keeps the scope path of every instruction in its ``op_name``, and
the profiler puts it in the ``tf_op`` stat of the op's event metadata, e.g.
``jit(run)/while/body/score/cond/branch_1_fun/start/jit(argsort)/sort:``.
``ProfileData`` does not expose event metadata, so a minimal protobuf
wire-format reader takes ``tf_op`` from ``XSpace.planes[].event_metadata``
(and the stat names from ``stat_metadata``); the ops are the events of the
"XLA Ops" line of ``ProfileData``, joined to their metadata by event name.

Busy time is split exactly: the window is ``trace_reduce``'s, and each
instant in which an op runs goes to the innermost op running then, a
container (``while``, ``conditional``, ``call``) only where none of its
children runs.  The op's time goes to the innermost phase or subsystem name
on its ``tf_op`` path, else to ``unscoped`` (copies XLA adds carry no
``tf_op``; so does every op of a program without scopes).  A fusion carries
its root instruction's path, so its whole time goes to that scope.
"""
from __future__ import annotations

import functools
import glob
import heapq
import json
import os
import pathlib
import sys

import numpy as np

from trace_reduce import CONTAINER, DEVICE_PLANE, OPS_LINE, UNIT_SPANS, _host_spans, _union

PHASES = ("clock", "completions", "score", "start", "bookkeeping")
SUBSYSTEMS = ("availability", "workflow", "data", "transfers", "faults")
UNSCOPED = "unscoped"
PROGRAM_SPAN = "advance_sim"  # the program's host span around one segment


# -- protobuf wire format: only what XSpace's metadata needs ----------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: an int for a varint, bytes
    for a length-delimited field (fixed-width fields are skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_value(entry: bytes) -> bytes:
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def tf_ops(path: str) -> dict:
    """``{plane name: {event name: tf_op}}`` over the device planes.  A name
    that two metadata entries of one plane give different paths maps to
    None.  ``XSpace``: planes = 1; ``XPlane``: name = 2, event_metadata = 4,
    stat_metadata = 5; ``XEventMetadata``: name = 2, stats = 5;
    ``XStatMetadata``: id = 1, name = 2; ``XStat``: metadata_id = 1,
    str_value = 5, ref_value = 7 (the id of a stat metadata naming it)."""
    out = {}
    for field, plane in _fields(pathlib.Path(path).read_bytes()):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        if not DEVICE_PLANE.match(name):
            continue
        tf_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        ops: dict = {}
        for entry in events:
            ev_name, op = "", None
            for f, v in _fields(_map_value(entry)):
                if f == 2:
                    ev_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_id:
                        op = stat[5].decode() if 5 in stat else stat_names.get(stat.get(7))
            if ev_name in ops and ops[ev_name] != op:
                op = None
            ops[ev_name] = op
        out[name] = ops
    return out


def scope_of(tf_op: str | None) -> str:
    """The innermost phase or subsystem name on an op's scope path (its last
    component is the operation itself)."""
    if not tf_op:
        return UNSCOPED
    for part in reversed(tf_op.split("/")[:-1]):
        if part in PHASES or part in SUBSYSTEMS:
            return part
    return UNSCOPED


def _split(ops: list, w0: float, w1: float) -> tuple[dict, dict]:
    """``ops``: ``(start, end, name, tf_op, container)``.  Seconds of the
    union of the ops' intervals inside ``[w0, w1]`` by scope and by
    ``(name, tf_op)``, each instant given to the innermost op running then."""
    ops = [(max(a, w0), min(b, w1), n, p, c) for a, b, n, p, c in ops]
    ops = sorted((o for o in ops if o[1] > o[0]), key=lambda o: o[0])
    by_scope: dict = {}
    by_op: dict = {}
    heap: list = []  # innermost first: no container, then the latest start
    points = sorted({o[0] for o in ops} | {o[1] for o in ops})
    k = 0
    for t, t_next in zip(points, points[1:]):
        while k < len(ops) and ops[k][0] <= t:
            a, b, _, _, container = ops[k]
            heapq.heappush(heap, (container, -a, b, k))
            k += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        if not heap:
            continue
        _, _, name, tf_op, _ = ops[heap[0][3]]
        dt = (t_next - t) * 1e-9
        scope = scope_of(tf_op)
        by_scope[scope] = by_scope.get(scope, 0.0) + dt
        by_op[name, tf_op] = by_op.get((name, tf_op), 0.0) + dt
    return by_scope, by_op


def reduce_planes(planes, ops_by_plane: dict) -> dict:
    """``planes``: those of a ``jax.profiler.ProfileData``; ``ops_by_plane``:
    ``tf_ops`` of the same file.  Seconds, as means over the devices:
    ``scopes`` (which sum to ``trace_reduce``'s busy time), ``top_ops`` per
    scope (name, ``tf_op``, seconds), and ``reentry_idle_s``, device idle
    time inside the program's ``advance_sim`` spans, ``reentry_spans`` of
    them."""
    planes = list(planes)
    devices = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        names = ops_by_plane.get(plane.name, {})
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = ev.start_ns
                ops.append((a, a + ev.duration_ns, ev.name.split(" = ")[0],
                            names.get(ev.name), bool(CONTAINER.match(ev.name))))
        devices[plane.name] = ops
    if not any(devices.values()):
        return {}
    spans = _host_spans(planes, set(UNIT_SPANS) | {PROGRAM_SPAN})
    units = [(a, b) for a, b, n in spans if n in UNIT_SPANS]
    # the window of trace_reduce.reduce_planes, computed as it computes it
    if units:
        window = (float(min(a for a, _ in units)), float(max(b for _, b in units)))
    else:
        allv = [(a, b) for ops in devices.values() for a, b, *_ in ops]
        window = (float(min(a for a, _ in allv)), float(max(b for _, b in allv)))
    w0, w1 = window
    program = [(a, b) for a, b, n in spans if n == PROGRAM_SPAN and a >= w0 and b <= w1]
    n_dev = len(devices)
    scopes: dict = {}
    top: dict = {}
    idle = 0.0
    for ops in devices.values():
        by_scope, by_op = _split(ops, w0, w1)
        for s, sec in by_scope.items():
            scopes[s] = scopes.get(s, 0.0) + sec / n_dev
        for op, sec in by_op.items():
            d = top.setdefault(scope_of(op[1]), {})
            d[op] = d.get(op, 0.0) + sec / n_dev
        busy = _union(np.clip(np.array([(a, b) for a, b, *_ in ops], np.float64).reshape(-1, 2),
                              w0, w1))
        for a, b in program:
            inside = np.clip(busy, a, b)
            idle += ((b - a) - float((inside[:, 1] - inside[:, 0]).sum())) * 1e-9 / n_dev
    return dict(
        window_s=(w1 - w0) * 1e-9,
        scopes=scopes,
        top_ops={s: [[name, tf_op, sec] for (name, tf_op), sec in
                     sorted(d.items(), key=lambda kv: -kv[1])[:5]] for s, d in top.items()},
        reentry_idle_s=idle,
        reentry_spans=len(program),
    )


@functools.lru_cache(maxsize=4)
def _reduce_cached(path: str, mtime_ns: int, size: int) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, tf_ops(path))


def reduce_file(path: str) -> dict:
    """``reduce_planes`` of one trace file, decoded once per file."""
    st = os.stat(path)
    return _reduce_cached(str(path), st.st_mtime_ns, st.st_size)


def newest_trace() -> str | None:
    """The newest trace under the harness's ``out/trace`` (readers are given
    no path)."""
    import harness

    paths = glob.glob(os.path.join(str(harness.OUT_DIR / "trace"), "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def for_run(run) -> dict | None:
    """The split of the run's trace: the newest trace the harness wrote, and
    only if its window is the one the run's trace reduction used."""
    if not run["trace"]:
        return None
    path = newest_trace()
    if path is None:
        return None
    red = reduce_file(path)
    if not red or red["window_s"] != run["trace"]["window_s"]:
        return None
    return red


def phase_us(run, scope: str) -> float | None:
    """Device microseconds per traced engine round in ``scope``; None where
    the program names no phase (every op unscoped)."""
    n = run["counters"].get("rounds_traced")
    red = for_run(run)
    if red is None or not n or not any(s in red["scopes"] for s in PHASES):
        return None
    return red["scopes"].get(scope, 0.0) / n * 1e6


def reentry_idle_us(run) -> float | None:
    """Device idle microseconds inside each ``advance_sim`` span; None where
    the program opens no such span."""
    red = for_run(run)
    if red is None or not red["reentry_spans"]:
        return None
    return red["reentry_idle_s"] / red["reentry_spans"] * 1e6


if __name__ == "__main__":
    red = reduce_file(sys.argv[1])
    print(json.dumps(red, indent=1))
