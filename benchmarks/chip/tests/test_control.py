"""The comparison can fail.  The control (the reference in bfloat16, below
the float32 the configurations state) must break a limit of every cell, and
a run whose timed path is broken underneath must come out not correct:
a step that returns its state unchanged, half of the lanes left out, and an
answer altered where it is produced; and a boundary fault, ``<=`` made
``<`` in the program's arrival test, which no search over rounding ties may
excuse.  (No one-chip cell exchanges data between chips; the held four-chip
cell's exchange is left out in ``test_sharded.py``.)"""
import time

import jax
import numpy as np
import pytest

import control
import harness
from test_harness import HELD, tiny, use_traffic_of

CELLS = [w["name"] for w in HELD["workloads"] if w["chips"] == 1]


def _over_a_limit(found: dict, limits: dict) -> bool:
    return any(found[k] > lim for k, lim in limits.items())


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 7, 40_000])
def test_control_breaks_a_limit(workload, seed, monkeypatch):
    use_traffic_of(workload, monkeypatch)
    _, cfg, traffic = harness.cell_spec(HELD, workload)
    found = control.readings(tiny(cfg), traffic, seed, frames=10)
    assert _over_a_limit(found, traffic["limits"]), found


def _run(workload, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    use_traffic_of(workload, monkeypatch)
    return harness.run_cell(HELD, workload, 12345, 1.0, False, time.perf_counter(),
                            devices=jax.devices(), resize=tiny)


def _frames_cell():
    return "wlcg_day.exact"


def _ensemble_cell():
    return "whatif16.q60"


def test_frame_that_does_nothing_is_caught(tmp_path, monkeypatch):
    import repro.core

    monkeypatch.setattr(repro.core, "advance_sim", lambda handle, horizon: handle)
    assert _run(_frames_cell(), tmp_path, monkeypatch)["correct"] is False


def test_altered_answer_in_a_frame_is_caught(tmp_path, monkeypatch):
    import repro.core

    real = repro.core.advance_sim

    def altered(handle, horizon):
        h = real(handle, horizon)
        jobs = h.state.jobs
        i = int(np.argmax(np.asarray(jobs.site) >= 0))
        jobs = jobs._replace(site=jobs.site.at[i].set((jobs.site[i] + 1) % h.state.sites.capacity))
        return h._replace(state=h.state._replace(jobs=jobs))

    monkeypatch.setattr(repro.core, "advance_sim", altered)
    assert _run(_frames_cell(), tmp_path, monkeypatch)["correct"] is False


def test_batch_that_does_nothing_is_caught(tmp_path, monkeypatch):
    import repro.core

    real = repro.core.simulate_many

    def unchanged(sb, policy, key, **kw):
        res = real(sb, policy, key, **kw)
        jobs = res.jobs
        return res._replace(jobs=jobs._replace(
            state=jax.numpy.where(jobs.valid, 0, jobs.state),
            site=jax.numpy.full_like(jobs.site, -1)), rounds=res.rounds * 0)

    monkeypatch.setattr(repro.core, "simulate_many", unchanged)
    assert _run(_ensemble_cell(), tmp_path, monkeypatch)["correct"] is False


def test_half_the_lanes_left_out_is_caught(tmp_path, monkeypatch):
    import repro.core

    real = repro.core.simulate_many

    def half(sb, policy, key, **kw):
        res = real(sb, policy, key, **kw)
        K = res.rounds.shape[0]
        lost = jax.numpy.arange(K)[:, None] >= K // 2
        jobs = res.jobs
        return res._replace(jobs=jobs._replace(
            state=jax.numpy.where(lost & jobs.valid, 0, jobs.state)))

    monkeypatch.setattr(repro.core, "simulate_many", half)
    assert _run(_ensemble_cell(), tmp_path, monkeypatch)["correct"] is False


def test_altered_answer_in_a_lane_is_caught(tmp_path, monkeypatch):
    import repro.core

    real = repro.core.simulate_many

    def altered(sb, policy, key, **kw):
        res = real(sb, policy, key, **kw)
        return res._replace(jobs=res.jobs._replace(site=res.jobs.site.at[0, 0].add(1)))

    monkeypatch.setattr(repro.core, "simulate_many", altered)
    assert _run(_ensemble_cell(), tmp_path, monkeypatch)["correct"] is False


BOUNDARY_RUN = """
import json, sys
import numpy as np
sys.path[:0] = [{chip!r}, sys.argv[1]]
import jax
from repro.core import get_policy, simulate
import scenario
from reference import compare
from reference.gridsim import GridSim
from traffic import generators as gen

# one job (B) arrives exactly one quantum after the first (A): the round A
# opens has its clock at B's arrival, a comparison of two equal floats
sites = gen.atlas_platform(6, seed=1)
jobs = gen.panda_jobs(60, seed=3, duration=3600.0)
a = jobs["arrival"].astype(np.float32)
a = np.sort(a + np.float32(200.0))
a[0] = np.float32(100.25)
a[1] = np.float32(a[0] + np.float32(60.0))
jobs = dict(jobs, arrival=a)
lane = dict(jobs=jobs, sites=sites, data=None, avail=None)
j, s, kw = scenario.to_program(lane)
res = simulate(j, s, get_policy("panda_dispatch"), jax.random.PRNGKey(0), quantum=60.0,
               max_rounds=10**6)
t = jax.device_get(dict(state=res.jobs.state, site=res.jobs.site, t_start=res.jobs.t_start,
                        t_finish=res.jobs.t_finish, retries=res.jobs.retries,
                        preempted=res.jobs.preempted, xfer_src=res.jobs.xfer_src,
                        round=res.rounds))
pair = compare.lane_pair(lambda: GridSim(jobs, sites, quantum=60.0, tie_ulps=compare.TIE_ULPS), t)
out = compare.numbers([pair])
out.pop("first_difference")
print(json.dumps(out))
"""


def _boundary(tmp_path, mutate: bool) -> dict:
    import shutil
    import subprocess
    import sys

    src = tmp_path / "src"
    shutil.copytree(harness.ROOT / "src", src, ignore=shutil.ignore_patterns("__pycache__"))
    if mutate:
        engine = src / "repro" / "core" / "engine.py"
        text = engine.read_text()
        assert text.count("(jobs.arrival <= clock)") == 1
        engine.write_text(text.replace("(jobs.arrival <= clock)", "(jobs.arrival < clock)"))
    script = tmp_path / "boundary.py"
    script.write_text(BOUNDARY_RUN.format(chip=str(harness.CHIP)))
    r = subprocess.run([sys.executable, str(script), str(src)], capture_output=True, text=True,
                       timeout=600, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return __import__("json").loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mutate", [False, True], ids=["program", "arrival_lt_clock"])
def test_boundary_fault_is_not_excused_as_a_tie(mutate, tmp_path):
    """An exact equality is no rounding tie: the program as it is matches the
    reference with no tie flipped, and the program with ``<`` for ``<=`` in
    its arrival test breaks a limit of the cell."""
    limits = harness.cell_spec(HELD, _frames_cell())[2]["limits"]
    found = _boundary(tmp_path, mutate)
    assert found["ties_flipped"] == 0
    assert _over_a_limit(found, limits) is mutate, found
