"""The trace reduction, on planes built by hand (exact answers) and on a
small trace recorded on a TPU v5e (``data/frames.xplane.pb``, written by
``record_trace.py``)."""
import json
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

import trace_reduce

FIXTURE = pathlib.Path(__file__).parent / "data" / "frames.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_run", 0, 100)]),
        NS(name="XLA Ops", events=[_ev("a", 10, 20), _ev("b", 20, 20),   # overlap: 10..40
                                   _ev("a", 60, 10), _ev("c", 90, 30)]),  # 60..70, 90..120
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("frame", 0, 80), _ev("snapshot", 80, 40)])])
    return [host, dev]


def test_reduction_of_hand_built_planes():
    red = trace_reduce.reduce_planes(_planes())
    # window: the frame span on the host, 0..80 ns; busy 10..40 and 60..70
    assert red["window_s"] == pytest.approx(80e-9)
    assert red["busy_s"][0] == pytest.approx(40e-9)
    assert red["idle_share"] == pytest.approx(0.5)
    # gaps 0..10, 40..60, 70..80, all inside the frame span
    assert dict(red["idle_gaps"]) == pytest.approx({"frame": 40e-9})
    assert dict(red["device_ops"]) == pytest.approx({"a": 30e-9, "b": 20e-9, "c": 30e-9})


def test_explicit_window_and_innermost_span():
    red = trace_reduce.reduce_planes(_planes(), window=(0.0, 130.0))
    assert red["busy_s"][0] == pytest.approx(70e-9)
    # 120..130 lies in the snapshot span (80..120 ends at 120: outside) -> other;
    # 70..90 has its midpoint 80 in the snapshot span
    gaps = dict(red["idle_gaps"])
    assert gaps["snapshot"] == pytest.approx(20e-9)
    assert gaps["other"] == pytest.approx(10e-9)


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce_planes([_planes()[0]]) == {}


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(FIXTURE)).planes)
    red = trace_reduce.reduce_planes(planes)
    assert set(red["busy_s"]) == {0}
    assert 0 < red["busy_s"][0] <= red["window_s"]
    assert 0 <= red["idle_share"] < 1
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    # a second witness: the module executions on the same device cover the ops
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    mods = [(e.start_ns, e.start_ns + e.duration_ns) for line in dev.lines
            if line.name == "XLA Modules" for e in line.events]
    union = trace_reduce._union(np.array(mods, float))
    assert red["busy_s"][0] <= (union[:, 1] - union[:, 0]).sum() * 1e-9 * (1 + 1e-9)
    # the window is the frame spans on the host: three frames and two snapshots
    assert {name for name, _ in red["idle_gaps"]} == {"frame"}
    assert red["window_s"] == pytest.approx(0.028329439)
    assert red["busy_s"][0] == pytest.approx(0.008368209)
    assert all(name.startswith("%") and " " not in name for name, _ in red["device_ops"])
    json.dumps(red)
