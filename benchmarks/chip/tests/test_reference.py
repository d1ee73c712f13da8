"""The plain reference agrees with the engine on small seeded scenarios on
the CPU, one per mechanism the benchmark's comparison covers: the round
loop alone, the data hook (replica choice, WAN stage-in, cache-on-read with
LRU eviction), availability (outage preemption and queue bounce) at exact
order and at a 60 s quantum, and an ensemble run in shape buckets and
reassembled into caller order."""
import numpy as np
import pytest

import harness
import scenario
from reference import compare
from reference.gridsim import GridSim
from traffic import generators as gen

# XLA rewrites some float expressions (e.g. a / (b / c) into a * c / b
# around availability's speed scaling), so times may differ from the
# reference by a rounding step; discrete outcomes must not.
TIME_GAP = 1e-6


def _lane(S, J, *, data=None, avail=None, seed=5):
    sites = gen.atlas_platform(S, seed=1)
    jobs = gen.panda_jobs(J, seed=seed, duration=3600.0, n_datasets=data)
    lane = dict(jobs=jobs, sites=sites, data=None, avail=None)
    if data:
        bw, lat = gen.atlas_network(S, seed=0)
        size = gen.zipf_sizes(data, seed=3)
        cap = sites["memory"] * np.float32(3e8)  # small disks: eviction happens
        lane["data"] = dict(bw=bw, latency=lat, size=size, disk_cap=cap,
                            origin=gen.replica_origins(cap, data, seed=0))
    if avail:
        lane["avail"] = gen.flaky_calendar(S, np.arange(1, S, 3), horizon=4 * 3600.0,
                                           mtbf=1800.0, seed=7, max_windows=8)
    return lane


def _job_table(jobs, rounds):
    return dict(state=jobs.state, site=jobs.site, t_start=jobs.t_start, t_finish=jobs.t_finish,
                retries=jobs.retries, preempted=jobs.preempted, xfer_src=jobs.xfer_src,
                round=rounds)


@pytest.mark.parametrize("case,S,J,data,avail,quantum", [
    ("round_loop", 12, 1500, None, False, 0.0),
    ("data_cache_evict", 8, 1500, 50, False, 0.0),
    ("availability_exact", 12, 1500, None, True, 0.0),
    ("availability_quantum", 12, 2500, None, True, 60.0),
])
def test_frames_match_engine(case, S, J, data, avail, quantum):
    import jax
    from repro.core import advance_sim, get_policy, init_sim

    lane = _lane(S, J, data=data, avail=avail)
    jobs, sites, kw = scenario.to_program(lane)
    h = init_sim(jobs, sites, get_policy("panda_dispatch"), jax.random.PRNGKey(0),
                 quantum=quantum, max_rounds=10**7, **kw)
    ref = GridSim(lane["jobs"], lane["sites"], data=lane["data"], avail=lane["avail"],
                  quantum=quantum)
    pairs = []
    for horizon in (600.0, 1800.0, 3600.0, np.inf):
        h = advance_sim(h, horizon)
        ref.run_until(horizon)
        pairs.append((jax.device_get(_job_table(h.state.jobs, h.state.round)),
                      dict(ref.snapshot(), round=ref.rounds)))
    got = compare.numbers(pairs)
    assert got["rows_differing"] == 0, got["first_difference"]
    assert got["rounds_differing"] == 0, got["first_difference"]
    assert got["time_gap_rel"] <= TIME_GAP
    if data:
        assert (ref.last_access == -np.inf).any(), "no replica was evicted"
    if avail:
        assert ref.preempted.sum() > 0, "no job was preempted"


def test_bucketed_ensemble_matches_engine():
    import jax
    from repro.core import Scenario, availability_subsystem, get_policy, simulate_many, stack_scenarios

    cfg = dict(builder="ensemble", platform=dict(n_sites=10, seed=1), failure_rate=0.0,
               policy="panda_dispatch", topk=None,
               jobs=dict(seed_base=10, order_seed=12), arrival_span_s=3600.0,
               ensemble=dict(n_lanes=5, jobs_lo=200, jobs_hi=700, speed_lo=0.7, speed_hi=1.3),
               calendar=dict(every=3, offset_mod=2, mtbf_s=1800.0, horizon_s=14400.0, seed_base=100))
    lanes = harness.load("builders", cfg["builder"]).lanes(cfg, 3)
    subs = (availability_subsystem(),)
    scens = []
    for lane in lanes:
        jobs, sites, kw = scenario.to_program(lane)
        scens.append(Scenario(jobs, sites, {"availability": kw["availability"]}))
    res = simulate_many(stack_scenarios(scens, subsystems=subs, buckets=3),
                        get_policy("panda_dispatch"), jax.random.PRNGKey(1),
                        subsystems=subs, quantum=60.0, max_rounds=10**7)
    got = jax.device_get(_job_table(res.jobs, res.rounds))
    pairs = []
    for i, lane in enumerate(lanes):
        ref = GridSim(lane["jobs"], lane["sites"], avail=lane["avail"], quantum=60.0)
        ref.run_until(np.inf)
        n = lane["jobs"]["arrival"].shape[0]
        prog = {k: (v[i][:n] if np.ndim(v) > 1 else v[i]) for k, v in got.items()}
        pairs.append((prog, dict(ref.snapshot(), round=ref.rounds)))
    out = compare.numbers(pairs)
    assert out["rows_differing"] == 0 and out["rounds_differing"] == 0, out["first_difference"]
    assert out["time_gap_rel"] <= TIME_GAP


@pytest.mark.parametrize("quantum,S,J,seed", [(0.0, 12, 2500, 5), (60.0, 24, 4000, 10)])
def test_tie_search_admits_a_rounding_tie_and_nothing_else(quantum, S, J, seed):
    """A run that breaks one tie the other way is matched by the search; a
    run with one job moved to another site is not."""
    lane = _lane(S, J, avail=quantum > 0, seed=seed)

    def sim(flips=frozenset()):
        g = GridSim(lane["jobs"], lane["sites"], avail=lane["avail"], quantum=quantum,
                    tie_ulps=compare.TIE_ULPS)
        g.flips = flips
        return g

    probe = sim()
    probe.run_until(np.inf)
    assert probe.n_ties > 0, "the scenario meets no tie"
    base = dict(probe.snapshot(), round=probe.rounds)
    for k in range(probe.n_ties):  # a tie whose other side changes the outcome
        other = sim(frozenset([k]))
        other.run_until(np.inf)
        prog = dict(other.snapshot(), round=other.rounds)
        if not compare._same(prog, base):
            break
    else:
        pytest.fail("no tie changes the outcome")
    found = compare.numbers([compare.lane_pair(sim, prog)])
    assert found["rows_differing"] == 0 and found["rounds_differing"] == 0
    assert found["ties_flipped"] == 1

    moved = {k: v.copy() if hasattr(v, "copy") else v for k, v in prog.items()}
    j = int(np.flatnonzero(moved["site"] >= 0)[0])
    moved["site"][j] = (moved["site"][j] + 1) % S
    assert compare.numbers([compare.lane_pair(sim, moved)])["rows_differing"] > 0
