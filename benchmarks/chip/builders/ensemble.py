"""What-if lanes over one platform: lane ``i`` of ``n_lanes`` scales the
sites' speed, has its own job count (both spaced evenly between the
configuration's bounds) and its own flaky-site outage calendar.

Each lane's job order is fixed by the configuration: in what-if lanes it
decides which jobs outages preempt, and so the work of a batch (a seeded
order moved a batch's time by up to 8%, measured on the chip, PR 12).
``--seed`` reorders the lanes.
"""
from __future__ import annotations

import numpy as np

import scenario
from traffic import generators as gen


def lanes(cfg: dict, seed: int, copies: int = 1) -> list[dict]:
    """``copies`` times the configuration's lanes, each copy with its own
    job orders, in an order drawn from ``seed``."""
    scenario.check_modelled(cfg)
    if "data" in cfg:
        raise ValueError("what-if lanes run without the data subsystem")
    plat = cfg["platform"]
    S = plat["n_sites"]
    sites = gen.atlas_platform(S, seed=plat["seed"], fail_rate=cfg["failure_rate"])
    jc, ens, cal = cfg["jobs"], cfg["ensemble"], cfg["calendar"]
    n = ens["n_lanes"]
    sizes = np.linspace(ens["jobs_lo"], ens["jobs_hi"], n).astype(int)
    speed = np.linspace(ens["speed_lo"], ens["speed_hi"], n)

    def flaky(i):
        return np.arange(i % cal["offset_mod"], S, cal["every"])

    w = max(gen.calendar_windows(S, flaky(i), horizon=cal["horizon_s"], mtbf=cal["mtbf_s"],
                                 seed=cal["seed_base"] + i) for i in range(n))
    out = []
    for c in range(copies):
        for i in range(n):
            jobs = gen.panda_jobs(int(sizes[i]), seed=jc["seed_base"] + i,
                                  duration=cfg["arrival_span_s"], **jc.get("shape", {}))
            lane_sites = dict(sites, speed=(sites["speed"] * np.float32(speed[i])).astype(np.float32))
            avail = gen.flaky_calendar(S, flaky(i), horizon=cal["horizon_s"], mtbf=cal["mtbf_s"],
                                       seed=cal["seed_base"] + i, max_windows=w)
            out.append(dict(jobs=gen.permute_jobs(jobs, scenario.lane_seed(jc["order_seed"], c, i)),
                            sites=lane_sites, data=None, avail=avail))
    return [out[k] for k in np.random.default_rng(scenario.lane_seed(seed)).permutation(len(out))]
