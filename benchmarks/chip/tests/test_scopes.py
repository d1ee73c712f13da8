"""The split of device time by the program's named scopes: the wire-format
reader on hand-built bytes, the split on hand-built planes (exact answers),
and two traces recorded on a TPU v5e: ``data/frames.xplane.pb`` (a program
without scopes, ``record_trace.py``) and ``data/phases.xplane.pb`` (with
them, ``record_phases.py``)."""
import pathlib
import shutil
from types import SimpleNamespace as NS

import pytest

import harness
import scopes
import trace_reduce

DATA = pathlib.Path(__file__).parent / "data"
FRAMES = DATA / "frames.xplane.pb"
PHASES = DATA / "phases.xplane.pb"


# -- protobuf wire format, written by hand ---------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _entry(key: int, value: bytes) -> bytes:
    return _int(1, key) + _len(2, value)


def _space() -> bytes:
    """One TPU plane: three op metadata entries, a ``tf_op`` as a string, one
    as a reference to a stat metadata naming it, one without, and fields the
    reader skips (a fixed64, a host plane)."""
    stats = [(7, "tf_op"), (8, "jit(run)/while/body/start/data/gather:"), (9, "flops")]
    plane = _len(2, b"/device:TPU:0") + _varint(7 << 3 | 1) + bytes(8)
    for sid, name in stats:
        plane += _len(5, _entry(sid, _int(1, sid) + _len(2, name.encode())))
    metas = [
        (1, "%fusion.1 = f32[] fusion()", _len(5, _int(1, 7) + _len(5, b"jit(run)/while/cond/clock/lt:"))),
        (2, "%gather.2 = f32[] gather()", _len(5, _int(1, 7) + _int(7, 8))),
        (3, "%copy.3 = f32[] copy()", _len(5, _int(1, 9) + _int(3, 12))),
    ]
    for mid, name, stat in metas:
        plane += _len(4, _entry(mid, _int(1, mid) + _len(2, name.encode()) + stat))
    host = _len(2, b"/host:CPU") + _len(4, _entry(1, _len(2, b"%fusion.1 = f32[] fusion()")))
    return _len(1, host) + _len(1, plane) + _len(4, b"hostname")


def test_wire_format_reader(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space())
    assert scopes.tf_ops(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": "jit(run)/while/cond/clock/lt:",
        "%gather.2 = f32[] gather()": "jit(run)/while/body/start/data/gather:",
        "%copy.3 = f32[] copy()": None}}


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(run)/while/body/score/cond/branch_1_fun/start/jit(argsort)/sort:", "start"),
    ("jit(run)/while/body/start/data/gather:", "data"),
    ("jit(run)/while/cond/clock/lt:", "clock"),
    ("jit(run)/while/body/concatenate:", "unscoped"),
    ("jit(run)/while/body/clock:", "unscoped"),  # the last component is the op itself
    (None, "unscoped"),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


# -- the split on hand-built planes ----------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes():
    ops = [_ev("%while.1 = while()", 0, 100),       # container over 0..100
           _ev("%a = f32[] a()", 10, 20),           # score, 10..30
           _ev("%b = f32[] b()", 20, 30),           # data, 20..50: innermost from 20
           _ev("%conditional.2 = c()", 60, 30),     # container over 60..90
           _ev("%c = f32[] c()", 70, 10),           # no tf_op, 70..80
           _ev("%d = f32[] d()", 110, 20)]          # after the window
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("frame", 0, 100), _ev("advance_sim", 2, 10), _ev("advance_sim", 85, 30),
        _ev("snapshot", 100, 20)])])
    names = {"/device:TPU:0": {"%a = f32[] a()": "jit(run)/while/body/score/mul:",
                               "%b = f32[] b()": "jit(run)/while/body/start/data/gather:",
                               "%d = f32[] d()": "jit(run)/while/body/clock/min:"}}
    return [host, dev], names


def test_split_of_hand_built_planes():
    planes, names = _planes()
    red = scopes.reduce_planes(planes, names)
    base = trace_reduce.reduce_planes(planes)
    assert red["window_s"] == base["window_s"] == pytest.approx(100e-9)
    # 0..10, 50..60, 60..70, 80..90, 90..100: containers alone -> unscoped;
    # 70..80: an op without tf_op -> unscoped
    assert red["scopes"] == pytest.approx({"score": 10e-9, "data": 30e-9, "unscoped": 60e-9})
    assert sum(red["scopes"].values()) == pytest.approx(base["busy_s_mean"])
    # no idle time in the window: the second advance_sim ends after it, so only
    # the first counts
    assert red["reentry_spans"] == 1 and red["reentry_idle_s"] == 0.0
    assert red["top_ops"]["data"] == [["%b", "jit(run)/while/body/start/data/gather:",
                                       pytest.approx(30e-9)]]


def test_idle_inside_program_spans():
    planes, names = _planes()
    dev = planes[1]
    dev.lines[0].events = [e for e in dev.lines[0].events if e.name.startswith(("%a", "%b"))]
    red = scopes.reduce_planes(planes, names)
    # busy 10..50; advance_sim 2..12 is idle over 2..10
    assert red["reentry_spans"] == 1
    assert red["reentry_idle_s"] == pytest.approx(8e-9)


# -- the chip traces -------------------------------------------------------

def test_program_without_scopes():
    """``frames.xplane.pb``: ops carry their scope path, none names a phase."""
    ops = scopes.tf_ops(str(FRAMES))["/device:TPU:0"]
    name = next(n for n in ops if n.startswith("%pad_add_fusion.8 "))
    assert ops[name] == "jit(run)/while/body/concatenate:"
    red = scopes.reduce_file(str(FRAMES))
    base = trace_reduce.reduce_file(str(FRAMES))
    assert set(red["scopes"]) == {"unscoped"}
    assert red["scopes"]["unscoped"] == pytest.approx(base["busy_s_mean"], abs=1e-9)
    assert red["reentry_spans"] == 0
    # busy time of ops (containers excluded) that carry a scope path
    from jax.profiler import ProfileData

    dev = next(p for p in ProfileData.from_file(str(FRAMES)).planes if p.name == "/device:TPU:0")
    with_path = total = 0.0
    for line in dev.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                if not trace_reduce.CONTAINER.match(ev.name):
                    total += ev.duration_ns
                    with_path += ev.duration_ns if ops.get(ev.name) else 0.0
    assert with_path >= 0.97 * total


def test_phases_partition_busy_time():
    """``phases.xplane.pb``: the phases and ``unscoped`` add up to busy time."""
    red = scopes.reduce_file(str(PHASES))
    base = trace_reduce.reduce_file(str(PHASES))
    assert red["window_s"] == base["window_s"]
    assert sum(red["scopes"].values()) == pytest.approx(base["busy_s_mean"], abs=1e-9)
    assert set(red["scopes"]) <= set(scopes.PHASES) | {"data", "unscoped"}
    assert set(scopes.PHASES) <= set(red["scopes"])
    assert red["scopes"].get("data", 0) > 0
    assert red["scopes"].get("unscoped", 0.0) <= 0.05 * base["busy_s_mean"]
    # one advance_sim span per frame, each inside its frame span
    assert red["reentry_spans"] == 3 and red["reentry_idle_s"] > 0


def _run(window_s, rounds=100):
    return dict(counters=dict(rounds_traced=rounds),
                trace=dict(busy_s_mean=1.0, window_s=window_s), window_s=1.0)


@pytest.mark.parametrize("fixture", [FRAMES, PHASES])
def test_readers(fixture, tmp_path, monkeypatch):
    """The readers read the newest trace under the harness's ``out/trace``,
    only where its window is the run's; a program without scopes or spans
    reads nothing."""
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    names = ["phase_us." + s + ".wlcg" for s in scopes.PHASES + ("data",)]
    names.append("reentry_idle_us.wlcg")
    window = scopes.reduce_file(str(fixture))["window_s"]
    for name in names:  # no trace yet
        assert harness.read_metric(name, _run(window)) is None
    (tmp_path / "trace" / "cell").mkdir(parents=True)
    shutil.copy(fixture, tmp_path / "trace" / "cell" / "t.xplane.pb")
    for name in names:
        assert harness.read_metric(name, _run(window * 1.5)) is None
        assert harness.read_metric(name, dict(counters={}, trace={}, window_s=1.0)) is None
    got = {n: harness.read_metric(n, _run(window)) for n in names}
    if fixture == FRAMES:
        assert set(got.values()) == {None}
        return
    red = scopes.reduce_file(str(fixture))
    phases = sum(got["phase_us." + s + ".wlcg"] for s in scopes.PHASES + ("data",))
    unscoped = red["scopes"].get("unscoped", 0.0) / 100 * 1e6
    busy = trace_reduce.reduce_file(str(fixture))["busy_s_mean"]
    assert phases + unscoped == pytest.approx(busy / 100 * 1e6, rel=1e-9)
    assert got["reentry_idle_us.wlcg"] == pytest.approx(red["reentry_idle_s"] / 3 * 1e6)
